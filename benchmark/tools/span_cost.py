"""What one ``Tracer.span()`` costs the host, in nanoseconds a record: ring
only, with the worker's file, with JAX loaded (the span also enters a
``TraceAnnotation``), and with a profiler session open. Spans are made as
the training loop makes them: ten inside one, the unit the tracer writes
at once; ``file_alone_ns`` is a span by itself, one write each.

    JAX_PLATFORMS=cpu python3 -m benchmark.tools.span_cost [records]

A host-side figure in a loop that does nothing else, so a floor: inside a
real step the same record costs several times more (cold caches, other
threads). It needs no chip and says nothing about one.
"""

import json
import os
import sys
import tempfile
import time

INSIDE = 10


def loop(tracer, n: int, inside: int = INSIDE) -> float:
    """ns a record over ``n`` records, ``inside`` spans in each outer."""
    steps = n // (inside + 1)
    t0 = time.perf_counter()
    for i in range(steps):
        with tracer.span("trainer.step", step=i):
            for _ in range(inside):
                with tracer.span("trainer.input"):
                    pass
    return (time.perf_counter() - t0) / (steps * (inside + 1)) * 1e9


def main(argv) -> int:
    from dlrover_tpu.utils.tracing import Tracer

    n = int(argv[0]) if argv else 100_000
    work = tempfile.mkdtemp(prefix="span_cost_")
    worker = dict(DLROVER_TPU_TRACE_FILE=os.path.join(work, "t.json"),
                  DLROVER_TPU_LOCAL_RANK="0", DLROVER_TPU_RESTART_COUNT="0")
    for name in worker:
        os.environ.pop(name, None)
    out = {"records": n, "ring_ns": loop(Tracer(), n)}
    os.environ.update(worker)
    out["file_ns"] = loop(Tracer(), n)
    out["file_alone_ns"] = loop(Tracer(), n // 4, inside=0)
    assert "jax" not in sys.modules
    import jax

    del os.environ["DLROVER_TPU_TRACE_FILE"]
    out["ring_jax_ns"] = loop(Tracer(), n)
    os.environ.update(worker)
    out["file_jax_ns"] = loop(Tracer(), n)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(os.path.join(work, "trace"),
                             profiler_options=options)
    out["file_jax_profiler_ns"] = loop(Tracer(), n)
    jax.profiler.stop_trace()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
