"""The one generator of training traffic: a token dataset from a seed.

A job file's ``data`` group holds the parameters; a new mix is a new data
file, never new code here:

- ``sequences``   how many sequences of the job's length the dataset holds
- ``tokens``      ``{"distribution": "uniform"}`` or ``{"distribution":
                  "zipf", "a": 1.2}``: token ids, most frequent first, as
                  natural text has them
- ``documents``   ``{"mean_length": 600, "sigma": 1.0, "eos_id": 0}``:
                  documents of log-normal length, each ended by ``eos_id``
                  and packed back to back into the sequences (GPT-style
                  packing: attention crosses the boundary); left out, a
                  sequence is one document

The program receives only the batches an ``ElasticDataLoader`` draws from
this array. Numpy only.
"""

import numpy as np


def make_dataset(data: dict, sequence: int, vocab_size: int,
                 seed: int) -> np.ndarray:
    """``[sequences, sequence]`` int32 token ids, the same for one seed."""
    rng = np.random.default_rng(seed)
    n = int(data["sequences"]) * sequence
    tokens = data.get("tokens", {"distribution": "uniform"})
    kind = tokens["distribution"]
    if kind == "uniform":
        ids = rng.integers(0, vocab_size, n, dtype=np.int64)
    elif kind == "zipf":
        # Rank r drawn with weight r^-a, folded into the vocabulary.
        ids = (rng.zipf(float(tokens["a"]), n) - 1) % vocab_size
    else:
        raise ValueError(f"unknown token distribution {kind!r}")
    docs = data.get("documents")
    if docs:
        mean, sigma = float(docs["mean_length"]), float(docs["sigma"])
        mu = np.log(mean) - sigma * sigma / 2
        # Enough documents to cover n tokens, then cut.
        count = int(n / mean * 2) + 16
        ends = np.cumsum(
            np.maximum(1, rng.lognormal(mu, sigma, count).astype(np.int64))
        )
        ids[ends[ends < n]] = int(docs.get("eos_id", 0))
    return ids.astype(np.int32).reshape(int(data["sequences"]), sequence)


class TokenDataset:
    """Indexable, as ``ElasticDataLoader`` wants a dataset."""

    def __init__(self, array: np.ndarray):
        self._array = array

    def __len__(self) -> int:
        return len(self._array)

    def __getitem__(self, i: int) -> np.ndarray:
        return self._array[i]


def epochs(loader, sampler, batch_size: int, start_batches: int = 0):
    """Batches without end: epoch after epoch of ``loader``, each shuffled
    by ``sampler`` from its seed, starting ``start_batches`` in (where a
    resumed job carries on)."""
    epoch, skip = divmod(start_batches, sampler.size // batch_size)
    sampler.load_state_dict(
        {"epoch": epoch, "consumed": skip * batch_size}
    )
    while True:
        yield from loader
        epoch += 1
        sampler.set_epoch(epoch)
