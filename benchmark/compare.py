"""The comparison behind ``correct``: the system's gradients against the
reference's, and the verdicts over what a run recorded."""

import functools
import math


def agreement(system: dict, reference: dict) -> dict:
    """Cosine and norm ratio (system / reference) of the gradients with
    respect to the embedding and to the first block, each taken over all
    of the group's values as one vector.

    ``system`` is the program's gradient tree under the reference's names
    (``models/<family>.to_reference``), layers stacked; ``reference`` is
    ``{"embed", "layer0"}`` as ``reference/common.loss_and_grads`` gives.
    """
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=1)
    def stats(pairs, stacked):
        if stacked:     # the system's layers are stacked: take the first
            pairs = [(a[0], b) for a, b in pairs]
        pairs = [(a.astype(jnp.float32), b) for a, b in pairs]
        dot = sum(jnp.vdot(a, b) for a, b in pairs)
        na = sum(jnp.sum(jnp.square(a)) for a, _ in pairs)
        nb = sum(jnp.sum(jnp.square(b)) for _, b in pairs)
        return dot / jnp.sqrt(na * nb), jnp.sqrt(na / nb)

    embed = stats([(system["embed"], reference["embed"])], False)
    layer0 = stats([
        (system["layers"][k], g)
        for k, g in sorted(reference["layer0"].items())
    ], True)
    return {
        name: {"cosine": float(cosine), "norm_ratio": float(ratio)}
        for name, (cosine, ratio) in (("embed", embed), ("layer0", layer0))
    }


def judge_reference(ref: dict, first_step_loss) -> list:
    """Reasons the run disagrees with the reference (none: it agrees).
    ``ref`` is the worker's ``reference`` record."""
    tol, why = ref["tolerance"], []
    if first_step_loss is None or not math.isfinite(first_step_loss):
        return [f"no finite first-step loss ({first_step_loss})"]
    gap = abs(first_step_loss - ref["loss_ref_batch"])
    if gap > tol["loss_abs"]:
        why.append(
            f"first-step loss {first_step_loss} vs reference "
            f"{ref['loss_ref_batch']}: |gap| {gap:.4g} > {tol['loss_abs']}"
        )
    for group, a in ref["agreement"].items():
        lo, hi = tol[group]["norm_ratio"]
        if not a["cosine"] >= tol[group]["cosine_min"]:
            why.append(
                f"{group} gradient cosine {a['cosine']:.6f} < "
                f"{tol[group]['cosine_min']}"
            )
        if not lo <= a["norm_ratio"] <= hi:
            why.append(
                f"{group} gradient norm ratio {a['norm_ratio']:.4f} "
                f"outside [{lo}, {hi}]"
            )
    return why
