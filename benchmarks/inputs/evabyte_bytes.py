"""Windows of byte ids, uniform over the 256 bytes of the vocabulary the
configuration's model holds (``published.held.first_byte_id`` up to
``published.vocab_size - 1``: ids 64-319 in the cell; the ids before them are
special and no byte of a file is one), as floats: the instance contract
carries ids so, exactly (under 2^24).

As ``minicpm_sala_tokens.py``, for the ``evabyte`` configurations. The
harness hands ``make`` a shape and no configuration, so the range is that of
the configuration *of this kind* whose model takes the shape: a kind of its
own keeps this family's windows apart from the others', whatever each comes
to hold (PERF.md section 7 item 4 d)."""

import json
import os

import numpy as np

KIND = "evabyte_bytes"
_CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _range(shape: tuple) -> tuple:
    found = set()
    for name in sorted(os.listdir(_CONFIGS)):
        with open(os.path.join(_CONFIGS, name)) as f:
            doc = json.load(f)
        if doc.get("inputs", {}).get("kind") == KIND \
                and tuple(doc["model"]["input_shape"]) == tuple(shape):
            sizes = doc["published"]
            found.add((int(sizes["held"]["first_byte_id"]),
                       int(sizes["vocab_size"])))
    if len(found) != 1:
        raise ValueError(f"{KIND}: windows of shape {shape} belong to "
                         f"{len(found)} vocabularies ({sorted(found)})")
    return found.pop()


def make(n: int, shape: tuple, seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed % 2 ** 32)
    return rng.randint(*_range(shape), size=(n, *shape)).astype(np.float64)
