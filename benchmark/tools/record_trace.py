#!/usr/bin/env python3
"""Record the small TPU trace that ``tests/benchmark`` checks the
reduction on, and print what a trace holds.

    chiprun -- python3 benchmark/tools/record_trace.py gpt2-xl.steady
    chiprun --chips 4 -- python3 benchmark/tools/record_trace.py gpt2-xl.fsdp4

Runs a cell's job in this process (it holds the chip; no launcher) at toy
depth and width, traces four steps the way ``worker.py`` does, and writes
``chiprun_out/trace_<cell>.xplane.pb.gz`` plus ``..._describe.json``. Not
a measurement: a toy model's times mean nothing.
"""

import gzip
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, ROOT)

def main(name: str) -> int:
    import jax

    from benchmark import cells, traffic, xplane
    from benchmark.tools import toy_on_chip
    from benchmark.worker import make_optimizer
    from dlrover_tpu.accel import ParallelSpec
    from dlrover_tpu.train.data.device_prefetch import DevicePrefetchIterator
    from dlrover_tpu.train.trainer import Trainer

    cell = toy_on_chip.resolve_toy(name)
    config, job = cell["config"], cell["job"]
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    built = cells.family_module("models", cell["family"]).build(config, job)
    data = traffic.make_dataset(
        dict(job["data"], sequences=256), 256, config["vocab_size"], 0
    )
    batch = int(job["batch"])
    trainer = Trainer(
        built["module"], make_optimizer(job["optimizer"]), built["loss"],
        data[:batch], spec=ParallelSpec(**job["parallel"]),
        report_metrics=False,
    )
    out = os.path.join(ROOT, "chiprun_out")
    trace_dir = os.path.join(out, f"trace_{name}")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2

    class Annotated(DevicePrefetchIterator):
        def __next__(self):
            with jax.profiler.TraceAnnotation("bench.next_batch"):
                time.sleep(0.002)  # a gap the host can be blamed for
                return super().__next__()

    feed = Annotated(
        (data[i:i + batch] for i in range(0, len(data) - batch, batch)),
        trainer.batch_sharding,
    )
    trainer.fit(feed, steps=4, start_step=0)   # compile, warm up
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    result = trainer.fit(feed, steps=8, start_step=4)
    jax.profiler.stop_trace()
    print("loss", result["loss"])
    path = xplane.find_xplane(trace_dir)
    packed = os.path.join(out, f"trace_{name}.xplane.pb.gz")
    with open(path, "rb") as src, gzip.open(packed, "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(trace_dir)
    with open(os.path.join(out, f"trace_{name}_describe.json"), "w") as f:
        json.dump(xplane.describe(packed), f, indent=1)
    print(json.dumps(xplane.reduce(packed)["summary"], indent=1)[:6000])
    print("trace bytes", os.path.getsize(packed))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
