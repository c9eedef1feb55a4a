#!/usr/bin/env python3
"""How far the reference comparison moves when the system computes in a
lower precision than its job states, at the cell's real size.

    chiprun -- python3 benchmark/tools/precision_probe.py gpt2-xl.steady

Prints the ``reference`` record (loss, gradient cosine and norm ratio
against ``benchmark/reference``) three times: the job as stated, with
int8 MLP matmuls (``mlp_precision="int8"``), and with every weight rounded
to 8 bits (float8 e4m3) before the system's gradient is taken. The
tolerance in the reference file has to pass the first and fail the rest.
This process holds the chip; no launcher.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, ROOT)


class Printer:
    def __init__(self, variant):
        self.variant = variant

    def write(self, event, **fields):
        from benchmark import compare

        # Here the system's loss is of the sample, so is the reference's.
        fields["loss_ref_batch"] = fields["loss_ref_sample"]
        verdict = compare.judge_reference(fields, fields["loss_sys_sample"])
        print(json.dumps({"variant": self.variant, **fields,
                          "fails_because": verdict}), flush=True)


def main(name: str) -> int:
    import jax
    from benchmark import cells, traffic, worker
    from dlrover_tpu.accel import ParallelSpec
    from dlrover_tpu.train.trainer import Trainer

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    cell = cells.resolve(name, ROOT)
    config, job = cell["config"], cell["job"]
    family = cells.family_module("models", cell["family"])
    data = traffic.make_dataset(
        dict(job["data"], sequences=int(job["batch"])), int(job["sequence"]),
        config["vocab_size"], 0,
    )
    spec = ParallelSpec(**job["parallel"])

    def float8(loss):
        def rounded(module, params, batch):
            # reduce_precision, not a cast there and back: the TPU compiler
            # removes a pair of converts (xla_allow_excess_precision).
            return loss(module, jax.tree_util.tree_map(
                lambda x: jax.lax.reduce_precision(x, 4, 3), params
            ), batch)
        return rounded

    variants = {
        "as_stated": (job, lambda loss: loss),
        "int8_mlp": (dict(job, mlp_precision="int8"), lambda loss: loss),
        "float8_weights": (job, float8),
    }
    for variant, (vjob, wrap) in variants.items():
        built = family.build(config, vjob)
        built["loss"] = wrap(built["loss"])
        trainer = Trainer(
            built["module"], worker.make_optimizer(vjob["optimizer"]),
            built["loss"], data, spec=spec, report_metrics=False,
            rng=jax.random.PRNGKey(0),
        )
        worker.check_against_reference(
            dict(cell, job=vjob), family, built, trainer, spec, data,
            Printer(variant),
        )
        del trainer
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
