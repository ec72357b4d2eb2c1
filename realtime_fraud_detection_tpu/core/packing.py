"""Transfer packing: collapse a [B, ...] pytree into 3 contiguous buffers.

``jax.device_put`` of the 65-leaf ScoreBatch is 65 transfers plus per-leaf
handling on the host. Packing every float leaf into one f32[B, Wf] matrix,
every int leaf into i32[B, Wi] and every bool leaf into u8[B, Wb] turns the
microbatch transfer into three dense buffers — one logical h2d payload —
and the device-side unpack is free: XLA fuses the slice/reshape/cast
back-out into the consumers, so no extra HBM traffic. What the packing
saves per microbatch is not measured on local hardware.

This is the TPU-native analog of the reference's serde layer
(TransactionDeserializer.java / serialization.py): where the reference
encodes per-record JSON for Kafka hops, this packs per-microbatch dense
tensors for the PCIe/network hop — the hop that actually matters here.

The spec (treedef + per-leaf layout) is static and hashable, so jitted
consumers take it as a static argument and compile once per bucket shape.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import ml_dtypes
import numpy as np
from jax import tree_util

_BF16 = np.dtype(ml_dtypes.bfloat16)

# leaf dtype kind -> (blob name, transfer dtype)
_KIND_TO_BLOB = {
    "f": ("f32", np.float32),
    "i": ("i32", np.int32),
    "u": ("i32", np.int32),
    "b": ("u8", np.uint8),
}

BLOB_NAMES = ("f32", "i32", "u8", "bf16")


def _blob_for(dtype: np.dtype) -> Tuple[str, np.dtype]:
    """Blob assignment for one leaf dtype. bfloat16 leaves ride their own
    half-width blob — the caller opts a tensor into bf16 transfer by casting
    it before packing (e.g. the LSTM history, ~45% of the ScoreBatch bytes),
    halving its wire size on bandwidth-bound links."""
    if dtype == _BF16:
        return "bf16", _BF16
    return _KIND_TO_BLOB[dtype.kind]


class PackSpec:
    """Static, hashable description of a packed pytree.

    ``entries[k] = (blob, offset, tail_shape, dtype_str)`` for leaf k in
    tree-flatten order; ``widths[blob]`` is each blob's total column count.
    """

    __slots__ = ("treedef", "entries", "widths", "_hash")

    def __init__(self, treedef, entries: Tuple, widths: Tuple):
        self.treedef = treedef
        self.entries = entries
        self.widths = widths
        self._hash = hash((treedef, entries, widths))

    def __hash__(self) -> int:
        return self._hash

    def __eq__(self, other) -> bool:
        return (isinstance(other, PackSpec)
                and self.treedef == other.treedef
                and self.entries == other.entries
                and self.widths == other.widths)


def pack_tree(tree: Any) -> Tuple[Dict[str, np.ndarray], PackSpec]:
    """Host side: flatten a pytree of [B, ...] arrays into 3 dense blobs.

    Every leaf must share the leading batch dim B. Ints must fit in int32
    (the ScoreBatch contract: codes, hours, token ids). Returns
    ``({"f32": [B,Wf], "i32": [B,Wi], "u8": [B,Wb]}, spec)``; empty blobs
    are [B, 0] so the device function signature is static.
    """
    leaves, treedef = tree_util.tree_flatten(tree)
    if not leaves:
        raise ValueError("pack_tree: empty pytree")
    b = int(np.shape(leaves[0])[0])
    parts: Dict[str, list] = {name: [] for name in BLOB_NAMES}
    offsets = {name: 0 for name in BLOB_NAMES}
    empty_dtype = {"f32": np.float32, "i32": np.int32, "u8": np.uint8,
                   "bf16": _BF16}
    entries = []
    for leaf in leaves:
        arr = np.asarray(leaf)
        if arr.ndim == 0 or arr.shape[0] != b:
            raise ValueError(
                f"pack_tree: every leaf needs leading dim {b}, "
                f"got shape {arr.shape}")
        blob, cast = _blob_for(arr.dtype)
        if (blob == "i32" and arr.dtype.itemsize > 4 and arr.size
                and (arr.max() > np.iinfo(np.int32).max
                     or arr.min() < np.iinfo(np.int32).min)):
            # fail loudly rather than silently wrapping (e.g. a future
            # epoch-ms int64 field would otherwise corrupt features)
            raise ValueError(
                f"pack_tree: {arr.dtype} leaf exceeds int32 range "
                f"(min={arr.min()}, max={arr.max()}); the ScoreBatch "
                f"contract requires ints to fit in int32")
        tail = arr.shape[1:]
        width = int(math.prod(tail))
        parts[blob].append(
            np.ascontiguousarray(arr.reshape(b, width), dtype=cast))
        entries.append((blob, offsets[blob], tail, arr.dtype.name))
        offsets[blob] += width
    blobs = {
        name: (np.concatenate(p, axis=1) if p
               else np.zeros((b, 0), empty_dtype[name]))
        for name, p in parts.items()
    }
    spec = PackSpec(treedef, tuple(entries),
                    tuple(offsets[n] for n in BLOB_NAMES))
    return blobs, spec


def unpack_tree(blobs: Dict[str, Any], spec: PackSpec) -> Any:
    """Device side (jit-traceable): slice the blobs back into the pytree.

    Pure slice/reshape/cast — XLA fuses these into the consumers, so the
    unpack costs no extra memory traffic on the device.
    """
    leaves = []
    for blob, offset, tail, dtype_name in spec.entries:
        width = int(math.prod(tail))
        col = blobs[blob][:, offset:offset + width]
        col = col.reshape((col.shape[0],) + tuple(tail))
        leaves.append(col.astype(np.dtype(dtype_name)))
    return tree_util.tree_unflatten(spec.treedef, leaves)
