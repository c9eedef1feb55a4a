"""What a decoder family does not define for itself.

:mod:`.gpt` and :mod:`.llama` each define their block, norms, embedding
and head; both import from here, and neither imports the other:

- ``attention``: the causal attention a block runs under ``attn_impl``,
  over every earlier key or a sliding window of them;
- ``remat_policy`` / ``count_residuals``: what a remat'ed block keeps for
  its backward pass, and the counter that says so;
- ``run_blocks``: remat-wrap, then ``nn.scan`` or a Python loop over the
  blocks, then the mean of their auxiliary outputs. A layer pattern
  (more than one kind of block in a stack) enters here, as ``kinds``;
- ``Stage`` / ``run_pipeline``: the same blocks as chunks of a GPipe or
  circular schedule (``dlrover_tpu.accel.pipeline``);
- ``loss_fn`` / ``moe_loss_fn`` / ``counted_loss_fn``.

The names of the parameter trees are the callers' (GPT ``blocks`` /
``block_{i}``, Llama ``layers`` / ``layer_{i}``, a stage ``blocks`` /
``block_{i}``): written checkpoints read them.
"""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


def attention(q, k, v, cfg, window: int = 0):
    """Causal attention, over every earlier key or, with ``window``,
    over the ``window`` keys that end in the query's own (a sliding
    window). q,k,v: [B, S, H, D]."""
    sliding = None
    if window:
        from dlrover_tpu.ops.attention import AttentionMask

        if cfg.attn_impl not in ("xla", "pallas"):
            raise ValueError(
                "sliding-window attention runs under attn_impl xla or "
                f"pallas, not {cfg.attn_impl!r}"
            )
        sliding = AttentionMask(window=window, sliding=True)
    if cfg.attn_impl == "pallas":
        from dlrover_tpu.ops.attention import flash_attention

        count_residuals(cfg, q)
        return flash_attention(
            q, k, v, causal=None if sliding else True, mask=sliding,
            block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
        )
    if cfg.attn_impl == "ring":
        from dlrover_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, causal=True, axis_name="seq")
    if cfg.attn_impl == "ulysses":
        from dlrover_tpu.ops.ulysses import ulysses_attention

        return ulysses_attention(q, k, v, causal=True, axis_name="seq")
    scale = 1.0 / np.sqrt(cfg.head_dim)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = q.shape[1]
    mask = sliding.dense(s, s) if sliding else (
        jnp.tril(jnp.ones((s, s), dtype=bool))
    )
    logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    probs = probs.astype(cfg.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


#: The policies under which a remat'ed block keeps what its flash
#: forward kernel wrote (``ops.attention.RESIDUAL_NAMES``).
_KEEPS_KERNEL_RESIDUALS = ("dots", "dots_lite")


def remat_policy(cfg):
    """Duck-typed on ``remat_policy`` and ``attn_impl``. What a remat'ed
    block keeps for its backward pass, and how often the flash kernel's
    forward (``attn_impl="pallas"``) then runs a layer a step:

    - "nothing": keep nothing, recompute everything (min HBM); the
      kernel's forward runs twice;
    - "dots": keep matmul outputs (usual throughput/memory sweet spot).
      The kernel is the block's largest pair of matmuls but a
      ``pallas_call`` and no ``dot_general``, so its two outputs, the
      attention output and the rows' log-sum-exp, are kept by name
      (``ops.attention.RESIDUAL_NAMES``): the forward runs once;
    - "dots_lite": keep ONLY the two expensive tensors per block — the
      attention output and the post-activation FFN tensor (named via
      ``checkpoint_name``) — and recompute the cheap qkv projections.
      Under the kernel the attention output is kept under the kernel's
      own names with its log-sum-exp (in place of ``attn_out``, the same
      tensor reshaped), so the forward runs once;
    - "offload": keep matmul outputs in *host* memory — activations
      leave HBM between fwd and bwd (parity: the reference's
      ``selective_offloading_checkpoint.py``); XLA streams them back
      over DMA during the backward pass. The kernel's outputs are not
      among them: the forward runs twice.
    """
    policies = jax.checkpoint_policies
    if cfg.remat_policy == "offload":
        return policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host"
        )
    if cfg.remat_policy not in _KEEPS_KERNEL_RESIDUALS:
        return policies.nothing_saveable
    from dlrover_tpu.ops.attention import RESIDUAL_NAMES

    if cfg.remat_policy == "dots":
        return policies.save_from_both_policies(
            policies.checkpoint_dots,
            policies.save_only_these_names(*RESIDUAL_NAMES),
        )
    attn = RESIDUAL_NAMES if cfg.attn_impl == "pallas" else ("attn_out",)
    return policies.save_only_these_names(*attn, "ffn_act")


def count_residuals(cfg, q):
    """Where a block builds its attention over queries ``q``
    ``[B, S, H, D]``: if it is remat'ed and runs the flash kernel, raise
    the program's ``attn.residuals`` counter by the bytes of what the
    forward kernel writes for the backward ones, under whether the
    block's policy keeps them."""
    if cfg.remat and cfg.attn_impl == "pallas":
        from dlrover_tpu.ops import attention as kernel

        kernel.count_residuals(q, cfg.remat_policy in _KEEPS_KERNEL_RESIDUALS)


def run_blocks(block_cls, cfg, x, length, *, scanned_name, unrolled_prefix,
               kinds=None):
    """``length`` blocks ``block_cls(cfg)`` over ``x``, inside the calling
    module's ``@nn.compact`` method: each block remat'ed under
    ``cfg.remat``, stacked with ``nn.scan`` (one parameter tree
    ``scanned_name`` with a leading layer axis; compile time O(1) in
    depth) or, with ``scan_layers`` off, called in a loop (trees
    ``{unrolled_prefix}{i}``). ``kinds``: for each layer the keywords its
    block is built with beside ``cfg``; layers of one kind stack as
    above, layers whose kinds differ are called in a loop whatever
    ``scan_layers`` says. A block maps ``x`` to ``(x, aux)``, ``aux`` a
    scalar or a dict of scalars. Returns ``(x, mean of the blocks'
    aux)``, ``None`` for blocks that give none."""
    block = block_cls
    if cfg.remat:
        block = nn.remat(
            block_cls, prevent_cse=False, policy=remat_policy(cfg)
        )
    kinds = kinds or [{}] * length
    one_kind = all(kind == kinds[0] for kind in kinds)
    if cfg.scan_layers and one_kind:
        x, aux = nn.scan(
            block,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            length=length,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(cfg, name=scanned_name, **kinds[0])(x)
        return x, jax.tree_util.tree_map(jnp.mean, aux)
    auxes = []
    for i, kind in enumerate(kinds):
        x, aux = block(cfg, name=f"{unrolled_prefix}{i}", **kind)(x)
        if aux is not None:
            auxes.append(aux)
    if not auxes:
        return x, None
    return x, jax.tree_util.tree_map(
        lambda *a: jnp.mean(jnp.stack(a)), *auxes
    )


class Stage(nn.Module):
    """One pipeline chunk: ``num_layers / (stages * repeats)`` blocks.
    The ``make_stage`` body of ``accel.pipeline.Pipeline`` /
    ``CircularPipeline``. MoE chunks return ``(x, aux_mean)`` so the
    load-balance loss rides the pipeline carry."""

    cfg: Any
    block: Any  # the family's block class

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        per_stage = cfg.num_layers // (
            cfg.pipeline_stages * max(cfg.pipeline_repeats, 1)
        )
        x, aux = run_blocks(
            self.block, cfg, x, per_stage,
            scanned_name="blocks", unrolled_prefix="block_",
        )
        if cfg.num_experts > 0:
            return x, aux
        return x


def run_pipeline(block_cls, cfg, x):
    """The blocks as ``cfg.pipeline_stages`` stages of a GPipe schedule
    or, with ``pipeline_repeats > 1``, of the circular one, inside the
    calling module's ``@nn.compact`` method (parameter tree
    ``pipeline``). Returns ``(x, aux)`` as ``run_blocks`` does."""
    from dlrover_tpu.accel.pipeline import CircularPipeline, Pipeline

    circular = cfg.pipeline_repeats > 1
    pipe_cls = CircularPipeline if circular else Pipeline
    kw = (
        {"num_repeats": cfg.pipeline_repeats}
        if circular
        else {"has_aux": cfg.num_experts > 0}
    )
    out = pipe_cls(
        make_stage=lambda: Stage(cfg, block_cls, name="stage"),
        num_stages=cfg.pipeline_stages,
        num_microbatches=cfg.pipeline_microbatches,
        carry_axes=("batch", "seq", "embed"),
        name="pipeline",
        **kw,
    )(x)
    return out if cfg.num_experts > 0 else (out, None)


def loss_fn(logits, tokens, ignore_first: bool = True):
    """Next-token cross entropy; logits[B,S,V], tokens[B,S].

    Computed as logsumexp - target_logit so no [B,S,V] f32 log-prob
    tensor is materialized (the logsumexp reduction streams over the
    vocab axis — at GPT-2 vocab size the full logp would be the largest
    activation in the model)."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def counted_loss_fn(out, tokens):
    """Loss for a model that returns ``(logits, counters)``: the
    next-token cross entropy and the counters beside it, the
    ``(scalar, {name: scalar})`` that ``accel.make_train_step`` carries
    into the step's metrics. The counters weigh nothing in the loss."""
    logits, counters = out
    return loss_fn(logits, tokens), counters


def moe_loss_fn(out, tokens, aux_weight: float = 1e-2):
    """Loss for MoE models: ``out`` is ``(logits, aux)`` from a model
    with ``num_experts > 0``; adds the load-balance aux loss (Switch's
    1e-2 default weight)."""
    logits, aux = out
    return loss_fn(logits, tokens) + aux_weight * aux
