#!/usr/bin/env python3
"""Compile a cell's programs for a described v5e:2x2, without a chip.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot.py gpt2-xl.fsdp4 [step ref sys]

Says what lowers, partitions and fits (``memory_analysis()``), and which
kernels and collectives the compiler put in; nothing about time. The
topology is described when ``main`` or a caller asks, never at import:
one process at a time may load libtpu (tests/benchmark does it in a
module-scoped fixture).
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def v5e_2x2():
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    ).devices


def hlo_counts(text: str) -> dict:
    """Kernels and collectives of a compiled HLO module. The TPU compiler
    turns a reduce-scatter into a fusion that calls an
    ``all-reduce-scatter`` computation."""
    def ops(*names):
        return sum(text.count(f" {n}(") for n in names)

    return {
        "mosaic_calls": text.count('custom_call_target="tpu_custom_call"'),
        "all_gather": ops("all-gather", "all-gather-start"),
        "reduce_scatter": ops("reduce-scatter")
        + text.count("calls=%all-reduce-scatter"),
        "collective_permute": ops(
            "collective-permute", "collective-permute-start"
        ),
    }


def programs(cell: dict, devices) -> dict:
    """``{"step", "ref", "ref_losses", "sys"}``: functions that lower and
    compile the cell's train step, the reference's gradient and loss
    programs and the system's gradient program for ``devices``, on
    abstract arguments (nothing is placed or run)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import cells
    from benchmark.reference import common
    from benchmark.worker import make_optimizer
    from dlrover_tpu.accel import ParallelSpec
    from dlrover_tpu.accel.accelerate import make_train_step
    from dlrover_tpu.accel.mesh import create_mesh
    from dlrover_tpu.accel.sharding import state_shardings, unbox

    config, job = cell["config"], cell["job"]
    family = cells.family_module("models", cell["family"], cell["bench_dir"])
    reference = cells.family_module(
        "reference", cell["family"], cell["bench_dir"]
    )
    built = family.build(config, job)
    model, loss = built["module"], built["loss"]
    opt = make_optimizer(job["optimizer"])
    spec = ParallelSpec(**job["parallel"])
    mesh = create_mesh(
        spec.axes() or [("data", 1)], devices=devices[:spec.total]
    )
    rules = spec.rules(vocab_size=config["vocab_size"])
    b, s = int(job["batch"]), int(job["sequence"])
    tokens = jnp.zeros((b, s), jnp.int32)

    def init_fn(rng):
        params = model.init(rng, tokens)["params"]
        return {"params": params, "opt": opt.init(params),
                "step": jnp.zeros((), jnp.int32)}

    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    shardings = state_shardings(mesh, abstract, rules)
    batch_sharding = NamedSharding(mesh, P(dict(rules)["batch"], None))
    state = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        unbox(abstract), shardings,
    )
    batch = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=batch_sharding)
    sample_of = job.get("reference", {})
    n = int(sample_of.get("grad_sample_sequences", 1))
    length = int(sample_of.get("grad_sample_tokens", s))
    sample = jax.ShapeDtypeStruct(
        (n, length), jnp.int32,
        sharding=batch_sharding if n % spec.total == 0
        else NamedSharding(mesh, P()),
    )

    def step():
        return make_train_step(
            model, opt, loss, mesh, rules, shardings, batch_sharding
        ).lower(state, batch).compile()

    def ref():
        return jax.jit(lambda p, t: common.loss_and_grads(
            reference, family.to_reference(p), t, config
        )).lower(state["params"], sample).compile()

    def ref_losses():
        return jax.jit(lambda p, t: common.losses(
            reference, family.to_reference(p), t, config
        )).lower(state["params"], batch).compile()

    def system():
        def f(p, t):
            with mesh, nn.logical_axis_rules(list(rules)):
                return jax.value_and_grad(lambda q: loss(model, q, t))(p)

        return jax.jit(f).lower(state["params"], sample).compile()

    return {"step": step, "ref": ref, "ref_losses": ref_losses,
            "sys": system}


def main(argv) -> int:
    import time

    from benchmark import cells
    from dlrover_tpu.ops import interpret as interpret_mode

    interpret_mode.use_interpret = lambda: False   # as on the chip
    devices = v5e_2x2()
    todo = programs(cells.resolve(argv[0], ROOT), devices)
    for what in argv[1:] or ["step"]:
        t0 = time.time()
        compiled = todo[what]()
        m = compiled.memory_analysis()
        print(argv[0], what, f"compile {time.time() - t0:.1f}s",
              f"peak {m.peak_memory_in_bytes / 1e9:.2f}e9",
              f"arguments {m.argument_size_in_bytes / 1e9:.2f}e9",
              hlo_counts(compiled.as_text()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
