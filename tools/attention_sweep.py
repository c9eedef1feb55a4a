"""Time the flash-attention kernels at the cells' shapes on the chip, under
each sub-tile size (``ops.attention._SUB_TILE``), beside other checkouts'
kernels where some are given:

    chiprun -- python3 tools/attention_sweep.py [--other .bench_tree/parent,...] [--tiles 128,256,512]

One JSON line a (shape, variant): milliseconds of the forward call and of
the two backward calls together, and the largest difference of the outputs
from the first variant's. Not a benchmark cell: a tool for choosing the
constant; the cells' traces say what a choice is worth in a step. A time
comes from a chip only: without a TPU the tool refuses to run.
"""

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dlrover_tpu.ops import attention  # noqa: E402
from dlrover_tpu.ops.attention import AttentionMask  # noqa: E402
from dlrover_tpu.ops.eva import eva_mask  # noqa: E402

# name: batch x heads, sequence, head width, mask, summary rows. The heads
# are folded into the batch, so the re-layouts around the kernels are
# reshapes and the times are the kernels' own.
SHAPES = {
    "gpt2-xl.b32s1k": (800, 1024, 64, AttentionMask(), 0),
    "mistral.s16k": (32, 16384, 128, AttentionMask(), 0),
    "trinity.sliding16k": (48, 16384, 128,
                           AttentionMask(window=4096, sliding=True), 0),
    "evabyte.s32k": (32, 32768, 128, eva_mask(32768, 2048, 16, 1024), 2048),
}


BLOCK = 1024     # the cells' block
REPS = 10


def _other(path):
    spec = importlib.util.spec_from_file_location(
        "attention_" + os.path.basename(path),
        f"{path}/dlrover_tpu/ops/attention.py",
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _ms(fn, args):
    out = fn(*args)
    jax.block_until_ready(out)
    start = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - start) / REPS, out


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--other", default="",
                        help="checkouts whose kernels run beside this one's")
    parser.add_argument("--tiles", default="128,256,512")
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"a {device.platform} gives no kernel times: run on the chip")
    variants = [(os.path.basename(path), _other(path), None)
                for path in args.other.split(",") if path]
    variants += [(f"t{tile}", attention, int(tile))
                 for tile in args.tiles.split(",")]
    for name, (b, s, d, mask, extra) in SHAPES.items():
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, g = (jax.random.normal(key, (b, s, 1, d), jnp.bfloat16)
                for key in keys[:2])
        k, v = (jax.random.normal(key, (b, s + extra, 1, d), jnp.bfloat16)
                for key in keys[2:])
        first = None
        for label, module, tile in variants:
            if tile:
                module._SUB_TILE = tile
            # each checkout's kernels read their own description
            mask = module.AttentionMask(**dataclasses.asdict(mask))
            fwd = jax.jit(lambda q, k, v: module._flash_fwd(
                q, k, v, mask, BLOCK, BLOCK, False))
            bwd = jax.jit(lambda q, k, v, o, lse, g: module._flash_bwd(
                mask, BLOCK, BLOCK, False, (q, k, v, o, lse), g))
            try:
                fwd_ms, (o, lse) = _ms(fwd, (q, k, v))
                bwd_ms, grads = _ms(bwd, (q, k, v, o, lse, g))
            except jax.errors.JaxRuntimeError as e:   # Mosaic refuses the size
                print(json.dumps({"shape": name, "variant": label,
                                  "error": str(e)[-600:]}), flush=True)
                continue
            outs = [x.astype(jnp.float32) for x in (o, *grads)]
            first = first or outs
            print(json.dumps({
                "shape": name, "variant": label, "fwd_ms": round(fwd_ms, 3),
                "bwd_ms": round(bwd_ms, 3),
                "max_diff": [float(jnp.max(jnp.abs(a - b)))
                             for a, b in zip(outs, first)],
                "device": device.device_kind,
            }), flush=True)


if __name__ == "__main__":
    main()
