"""Host-offloaded optimizer — opt state at rest in host memory.

Capability parity with the reference's CPU-offloaded Adam
(``atorch/atorch/optimizers/adam_offload.py:309``: moments pinned in host
RAM, only params/grads/updates cross PCIe). The TPU-first version needs
no custom kernel: XLA memory spaces do the whole job —

- the jitted train step's in/out shardings pin the optimizer state to
  the ``pinned_host`` memory space, so between steps (the entire
  forward/backward, where the activation peak lives) the moments occupy
  ZERO HBM;
- inside the step, the wrapper explicitly streams the state
  host→device around the wrapped transform's update and back
  (``jax.device_put`` with memory-kind shardings — XLA schedules the
  per-leaf transfers).

Peak HBM becomes ``max(fwd/bwd peak without opt state, update peak
without activations)`` — the same trade the reference's offloaded Adam
makes, minus the custom CPU kernel. An opt-in ``host_compute`` mode
additionally runs the update math itself on the host CPU via
``compute_on("device_host")`` so the moments never touch HBM at all;
it is not the default because XLA's host-region placement annotations
do not yet compose with every SPMD program (scalar side-effect ops lose
their sharding — spmd_partitioner RET_CHECK).

Composes with any optax transform (adamw, the 8-bit adam, bf16 master);
use via ``auto_accelerate(..., offload_optimizer=True)``, which wires
the shardings on the jitted step.
"""

from typing import Optional

import jax
import optax

__all__ = [
    "offload",
    "offload_shardings",
    "normalize_shardings",
    "host_memory_kind_supported",
    "activation_offload_supported",
]

_HOST_KIND = "pinned_host"
_MIN_OFFLOAD_ELEMS = 4096


def host_memory_kind_supported(device=None) -> bool:
    """True if this backend exposes the pinned-host memory space."""
    import jax.numpy as jnp

    dev = device if device is not None else jax.devices()[0]
    try:
        s = jax.sharding.SingleDeviceSharding(dev, memory_kind=_HOST_KIND)
        jax.device_put(jnp.zeros((1,)), s)
        return True
    except Exception:
        return False


def activation_offload_supported(device=None) -> bool:
    """True if the backend can *execute* an offloading remat policy
    (the ``annotate_device_placement`` custom call inside a checkpointed
    region; TPU yes, the CPU test backend currently no)."""
    import jax.numpy as jnp

    policy = jax.checkpoint_policies.offload_dot_with_no_batch_dims(
        "device", _HOST_KIND
    )

    from jax import lax

    @jax.jit
    def probe(x, ws):
        # Mirror the real model shape: a scan of checkpointed layers,
        # so offloaded residuals must survive the loop (simpler probes
        # get elided on backends that fail real models).
        def layer(y, w):
            return jnp.tanh(y @ w), None

        def f(y):
            out, _ = lax.scan(
                jax.checkpoint(layer, policy=policy), y, ws
            )
            return out

        return jax.grad(lambda y: f(y).sum())(x)

    try:
        ws = jnp.ones((2, 256, 256))
        probe(jnp.ones((256, 256)), ws).block_until_ready()
        return True
    except Exception:
        return False


def offload_train_supported(device=None) -> bool:
    """True if the backend can *execute* a jitted step whose state lives
    in host memory with explicit cross-space transfers (TPU yes; the
    CPU test backend hoists the producing ops onto host placements its
    runtime cannot run)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P, Mesh
    import numpy as np

    dev = device if device is not None else jax.devices()[0]
    if not host_memory_kind_supported(dev):
        return False
    try:
        mesh = Mesh(np.array([dev]), ("d",))
        host = NamedSharding(mesh, P(), memory_kind=_HOST_KIND)
        devs = NamedSharding(mesh, P())

        def step(s, g):
            s_dev = jax.device_put(s, devs)
            out = s_dev * 0.9 + g
            return jax.device_put(out, host), (g * 2).sum()

        f = jax.jit(step, in_shardings=(host, devs),
                    out_shardings=(host, devs))
        s0 = jax.device_put(jnp.zeros((8192,)), host)
        jax.block_until_ready(f(s0, jnp.ones((8192,))))
        return True
    except Exception:
        return False


def _truncate_spec(s, a):
    """Rebuild a NamedSharding with its spec truncated to the leaf's
    rank: default-kind shardings tolerate over-long specs, memory-kind
    ones are validated strictly (and an opt state may inherit the spec
    of a param of higher rank)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if not isinstance(s, NamedSharding) or not hasattr(a, "ndim"):
        return s
    return NamedSharding(s.mesh, P(*tuple(s.spec)[: a.ndim]))


def _offloadable(a) -> bool:
    """Worth (and safe to) move: a plain array leaf of real size. A
    composite subtree under one prefix sharding (the quantized adam's
    _QTensor: q and scale behind one spec, the scale's last two axes
    the param's swapped) cannot take a strictly-validated memory-kind
    sharding — and its whole point is already being tiny, so it stays
    on device."""
    if not hasattr(a, "ndim"):
        return False
    return a.ndim > 0 and a.size >= _MIN_OFFLOAD_ELEMS


def normalize_shardings(opt_shardings, abstract_opt):
    """Rank-truncate every spec (device memory kind; see
    ``_truncate_spec``). ``abstract_opt`` is flattened up to the
    shardings tree, so prefix shardings (one spec over a composite
    subtree) pass through untouched."""
    return jax.tree_util.tree_map(
        lambda s, a: _truncate_spec(s, a), opt_shardings, abstract_opt
    )


def offload_shardings(opt_shardings, abstract_opt=None):
    """Host-memory-kind shardings for the big optimizer-state leaves.

    Small leaves (adam step counts, bias moments, quantization scales)
    stay on device: they carry no memory worth saving, and the SPMD
    partitioner rejects placement annotations on unsharded scalars.
    """

    def move(s, a=None):
        s = _truncate_spec(s, a)
        if a is not None and not _offloadable(a):
            return s
        try:
            return s.with_memory_kind(_HOST_KIND)
        except Exception:
            return s

    if abstract_opt is None:
        return jax.tree_util.tree_map(move, opt_shardings)
    return jax.tree_util.tree_map(move, opt_shardings, abstract_opt)


def offload(
    inner: optax.GradientTransformation,
    device_shardings=None,
    host_shardings=None,
    host_compute: bool = False,
) -> optax.GradientTransformation:
    """Wrap ``inner`` so its state streams host→device around the
    update (placement comes from the caller's jit shardings —
    ``auto_accelerate(..., offload_optimizer=True)`` wires both trees).

    ``host_compute=True`` instead runs the update inside a
    ``compute_on("device_host")`` region (operands stream
    automatically); opt-in, see module docstring.
    """
    from jax.experimental import compute_on

    moved = None
    if device_shardings is not None and host_shardings is not None:
        moved = jax.tree_util.tree_map(
            lambda d, h: getattr(h, "memory_kind", None) == _HOST_KIND,
            device_shardings, host_shardings,
        )

    def init(params):
        return inner.init(params)

    def _put(tree, shardings):
        if shardings is None or moved is None:
            return tree
        # shardings first: `tree` is flattened up to the (possibly
        # prefix) shardings structure, and only leaves that actually
        # changed memory space transfer — a no-op device_put on an
        # unsharded scalar would strand an unannotated placement
        # custom-call in the SPMD partitioner.
        return jax.tree_util.tree_map(
            lambda s, m, x: jax.device_put(x, s) if m else x,
            shardings, moved, tree,
        )

    def update(grads, state, params=None):
        if host_compute:
            with compute_on.compute_on("device_host"):
                return inner.update(grads, state, params)
        state = _put(state, device_shardings)
        updates, new_state = inner.update(grads, state, params)
        return updates, _put(new_state, host_shardings)

    return optax.GradientTransformation(init, update)
