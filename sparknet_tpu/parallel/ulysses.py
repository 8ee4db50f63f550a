"""Ulysses-style all-to-all sequence parallelism.

The second canonical long-context strategy next to ring attention
(DeepSpeed-Ulysses, Jacobs et al. 2023 — see PAPERS.md): instead of
rotating K/V shards around a ring, one ``all_to_all`` re-shards the
[B, H, S, D] tensors from sequence-sharded to head-sharded, every device
runs ordinary full-sequence attention for its head group, and a second
``all_to_all`` restores sequence sharding.

Trade-off vs the ring (why both exist):
- Ulysses moves each element twice over ICI but computes with plain dense
  attention — best when H >= n_devices and the full [S_local, S] score
  block fits HBM; the attention itself needs no online-softmax machinery,
  so any attention kernel (e.g. a pallas flash kernel) drops in unchanged.
- Ring keeps traffic to one neighbor hop per step and never materializes
  full-sequence scores — scales to sequences where even one head's full
  attention would not fit.

Requires ``num_heads % mesh_size == 0`` (each device owns H/n heads).
"""

from __future__ import annotations

from functools import partial

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparknet_tpu.parallel.mesh import shard_map as _shard_map


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False):
    """Inside-shard_map body: local blocks are [B, H, S/n, D].

    all_to_all #1: scatter heads / gather sequence -> [B, H/n, S, D];
    full attention per head group; all_to_all #2: scatter sequence /
    gather heads -> [B, H, S/n, D].
    """
    from sparknet_tpu.ops.pallas_kernels import flash_attention

    a2a = partial(jax.lax.all_to_all, axis_name=axis_name, tiled=True)
    # split the head axis across devices, concatenate the sequence axis
    qh, kh, vh = (a2a(x, split_axis=1, concat_axis=2) for x in (q, k, v))
    # local attention is pluggable: SPARKNET_ATTN_IMPL=pallas runs the
    # blocked flash kernel on the MXU; default is the XLA formulation
    oh = flash_attention(qh, kh, vh, causal=causal)
    # inverse: split sequence back out, concatenate heads home
    return a2a(oh, split_axis=2, concat_axis=1)


def ulysses_self_attention(
    mesh: Mesh,
    q,
    k,
    v,
    seq_axis: str = "seq",
    causal: bool = False,
):
    """shard_map wrapper mirroring :func:`ring_self_attention`:
    [B, H, S, D] arrays sharded on S over ``seq_axis``; output keeps the
    same sharding.  H must divide evenly by the mesh axis size."""
    n = mesh.shape[seq_axis]
    H = q.shape[1]
    if H % n != 0:
        raise ValueError(
            f"ulysses needs num_heads ({H}) divisible by the "
            f"{seq_axis!r} mesh axis size ({n}); use ring attention for "
            "head counts below the mesh size"
        )
    S = q.shape[2]
    if S % n != 0:
        raise ValueError(
            f"sequence length ({S}) must divide evenly over the "
            f"{seq_axis!r} mesh axis size ({n})"
        )
    spec = P(None, None, seq_axis, None)
    # a pallas_call inside the body can't annotate varying-mesh-axes on its
    # out_shape, which jax's vma check requires — disable the check ONLY
    # when the flash kernel is routed in; the default XLA path keeps it
    import os

    attn_impl = os.environ.get("SPARKNET_ATTN_IMPL", "xla")
    fn = _shard_map(
        partial(ulysses_attention, axis_name=seq_axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=attn_impl == "xla",
    )
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    return fn(q, k, v)
