"""Named-axis collective wrappers: the framework's ICI/DCN communication API.

The reference moves data between parallel workers via Kafka partitions and
Flink's keyBy shuffle (SURVEY.md §5.8). Inside a jitted TPU program the
equivalents are XLA collectives over the mesh axes; these thin wrappers pin
the axis-name conventions so call sites never hard-code strings.

All of these are valid only inside ``shard_map`` (or vmapped/pjit code with
manual axes) over a mesh built by ``core.mesh.build_mesh``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from realtime_fraud_detection_tpu.core.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS


def psum_data(x):
    """All-reduce over the data axis (gradient sync; the allreduce of DP)."""
    return jax.lax.psum(x, DATA_AXIS)


def pmean_data(x):
    return jax.lax.pmean(x, DATA_AXIS)


def psum_model(x):
    """All-reduce over the tensor-parallel axis (Megatron row-parallel sums)."""
    return jax.lax.psum(x, MODEL_AXIS)


def all_gather_seq(x, axis: int = 0):
    """Gather sequence shards (context-parallel rendezvous)."""
    return jax.lax.all_gather(x, SEQ_AXIS, axis=axis, tiled=True)


def _static_axis_size(axis: str) -> int:
    """Trace-time axis size as a Python int (needed for ppermute's static
    permutation and fori_loop trip counts). ``jax.lax.axis_size`` where it
    exists; on 0.4.x, read the axis environment the shard_map trace
    installed."""
    asz = getattr(jax.lax, "axis_size", None)
    if asz is not None:
        return asz(axis)
    from jax.core import axis_frame

    frame = axis_frame(axis)
    return int(getattr(frame, "size", frame))


def ppermute_seq(x, shift: int = 1):
    """Ring shift over the seq axis (ring attention's KV rotation)."""
    n = _static_axis_size(SEQ_AXIS)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, SEQ_AXIS, perm)


def reduce_scatter_data(x, axis: int = 0):
    """Reduce-scatter over data (ZeRO-style sharded gradient reduction)."""
    return jax.lax.psum_scatter(x, DATA_AXIS, scatter_dimension=axis, tiled=True)


def seq_index():
    return jax.lax.axis_index(SEQ_AXIS)


def seq_size():
    return _static_axis_size(SEQ_AXIS)


def shard_map_over(mesh: Mesh, fn, in_specs, out_specs, check_rep: bool = False):
    """``jax.shard_map`` pinned to this framework's mesh axis names — every
    collective call site goes through here."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)


def identity_spec() -> P:
    return P()
