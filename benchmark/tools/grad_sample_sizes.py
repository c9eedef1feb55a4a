#!/usr/bin/env python3
"""The gradient comparison of a cell at other sample lengths than its job
states, on the chip, at the published widths.

    chiprun -- python3 benchmark/tools/grad_sample_sizes.py \
        evabyte.train32k 8192 16384 32768

A cell holds its gradients against the plain reference on a sample cut to
``reference.grad_sample_tokens`` and times its kernels at ``sequence``.
Where a kernel's work is laid out per shape (the block schedule of a
windowed mask, summary rows padded to whole blocks at one length and not
at another), the sample's layout is not the timed one. This prints the
``reference`` record — loss, gradient cosine and norm ratio, and the
verdict under the family's TOLERANCE — once per length, same seed, same
parameters, through ``worker.check_against_reference``. A length that
does not fit beside the training state is reported and passed over.
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
    def write(self, event, **fields):
        from benchmark import compare

        # The system's loss is of the sample here, so is the reference's.
        fields["loss_ref_batch"] = fields["loss_ref_sample"]
        verdict = compare.judge_reference(fields, fields["loss_sys_sample"])
        print(json.dumps({**fields, "fails_because": verdict}), flush=True)


def main(name: str, lengths: list) -> int:
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
    built = family.build(config, job)
    trainer = Trainer(
        built["module"], worker.make_optimizer(job["optimizer"]),
        built["loss"], data, spec=spec, report_metrics=False,
        rng=jax.random.PRNGKey(0),
    )
    failed = 0
    for length in lengths:
        sized = dict(job, reference=dict(
            job.get("reference", {}), grad_sample_tokens=length
        ))
        try:
            worker.check_against_reference(
                dict(cell, job=sized), family, built, trainer, spec, data,
                Printer(),
            )
        except Exception as e:     # out of memory at this length: say so
            failed += 1
            print(json.dumps({
                "sample_shape": [int(job["batch"]), length],
                "error": f"{type(e).__name__}: {str(e)[:400]}",
            }), flush=True)
    return 1 if failed == len(lengths) else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], [int(x) for x in sys.argv[2:]]))
