#!/usr/bin/env python3
"""A cell's whole control flow on the chip at toy sizes: launcher, worker,
window, trace, kill and resume in well under a minute. It finds faults in
the harness before a full-size run is spent on them; what it prints is
not a measurement (a toy model's times mean nothing).

    chiprun -- python3 benchmark/tools/toy_on_chip.py --workload gpt2-xl.elastic --seconds 5 --trace 1

Takes ``run.py``'s arguments. The sizes are the rehearsal's, widened to
what the Mosaic kernels tile (head dimension 128).
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
sys.path.insert(0, ROOT)

from benchmark import cells, run  # noqa: E402

TOY = {
    "gpt2": {"vocab_size": 1024, "n_positions": 256, "n_ctx": 256,
             "n_embd": 256, "n_layer": 2, "n_head": 2},
    "mistral": {"vocab_size": 1024, "hidden_size": 256,
                "intermediate_size": 512, "num_hidden_layers": 2,
                "num_attention_heads": 2, "num_key_value_heads": 1,
                "head_dim": 128, "max_position_embeddings": 1024},
}
_resolve = cells.resolve


def resolve_toy(name, root=ROOT, rehearsal=False):
    cell = _resolve(name, root, rehearsal=True)
    cell["rehearsal"] = False          # the worker insists on a TPU
    cell["config"].update(TOY[cell["family"]])
    cell["job"].update(sequence=256, attention={
        "impl": "pallas", "block_q": 128, "block_k": 128,
    })
    cell["job"].setdefault("reference", {}).pop("grad_sample_tokens", None)
    return cell


if __name__ == "__main__":
    cells.resolve = resolve_toy
    sys.exit(run.main())
