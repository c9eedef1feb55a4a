"""Ulysses-style all-to-all sequence parallelism.

Capability parity with DeepSpeed-Ulysses (the reference integrates it as
the all-to-all alternative to its distributed attention,
``atorch/atorch/modules/distributed_transformer/``): instead of rotating
K/V blocks around a ring, ONE all-to-all re-shards the activations from
sequence-sharded to head-sharded, every device runs *full-sequence*
attention over its head group, and a second all-to-all restores the
sequence sharding.

Trade-offs vs the ring (``ops/ring_attention.py``):

- comm volume is 2 all-to-alls of the q/k/v/out activations —
  ``O(tokens*d)`` total, independent of the seq degree — versus the
  ring's ``(n-1)`` K/V hops; on all-to-all-friendly fabrics (ICI torus)
  Ulysses wins at high degrees;
- the head count must divide the seq degree's mesh axis (heads become
  the sharded dim during attention) — the ring has no such constraint;
- each device sees the FULL sequence during attention, so the inner
  kernel can be the Pallas flash kernel unchanged (``inner="pallas"``),
  while the ring needs its own online-softmax accumulation.

Both are exact; pick per topology. ``ulysses_attention`` falls back to
plain attention when the mesh has no ``seq`` axis, so model code can
enable it unconditionally (same contract as ``ring_attention``).
"""

import jax
import jax.numpy as jnp
from jax import lax

from dlrover_tpu.common.log import logger

__all__ = ["ulysses_attention", "ulysses_attention_shard"]


def ulysses_attention_shard(q, k, v, causal: bool = True,
                            axis_name: str = "seq",
                            inner: str = "xla"):
    """Per-device body (run under ``shard_map``).

    q, k, v: device-local seq blocks [B, S_local, H, D]; H must be
    divisible by the ``axis_name`` mesh size.
    """
    n = lax.psum(1, axis_name)
    b, s_loc, h, d = q.shape
    if h % n:
        raise ValueError(
            f"ulysses: heads {h} not divisible by seq degree {n}"
        )
    # seq-sharded -> head-sharded: split the head dim across the axis,
    # concatenate the sequence blocks. [B, S/n, H, D] -> [B, S, H/n, D]
    qg = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                        tiled=True)
    kg = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                        tiled=True)
    vg = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                        tiled=True)
    if inner == "pallas":
        from dlrover_tpu.ops.attention import flash_attention_shard

        out = flash_attention_shard(qg, kg, vg, causal=causal)
    else:
        from dlrover_tpu.ops.attention import reference_attention

        out = reference_attention(qg, kg, vg, causal=causal)
    # head-sharded -> seq-sharded.
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention(q, k, v, causal: bool = True,
                      axis_name: str = "seq", inner: str = "xla",
                      mesh=None):
    """Sequence-parallel attention via two all-to-alls over the ambient
    mesh's ``seq`` axis. q, k, v: GLOBAL [B, S, H, D] (seq-sharded by
    GSPMD). Falls back to plain attention without a ``seq`` axis."""
    from dlrover_tpu.ops.ring_attention import _ambient_mesh, _attn_specs

    mesh = mesh if mesh is not None else _ambient_mesh()
    if (
        mesh is None
        or axis_name not in mesh.axis_names
        or mesh.shape[axis_name] <= 1
    ):
        from dlrover_tpu.ops.attention import reference_attention

        logger.debug(
            "ulysses_attention: no %r mesh axis; using plain attention",
            axis_name,
        )
        return reference_attention(q, k, v, causal=causal)
    spec = _attn_specs(mesh, axis_name)
    fn = jax.shard_map(
        lambda a, b_, c: ulysses_attention_shard(
            a, b_, c, causal=causal, axis_name=axis_name, inner=inner
        ),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
