"""Windows of token ids, uniform over the vocabulary the configuration's
model holds (``model.num_classes`` rows: for ``ouro_2_6b`` the whole
published vocabulary of 49,152), as floats: the instance contract carries
ids so, exactly (under 2^24).

As ``falcon_h1_tokens.py`` and ``lfm2_tokens.py``, for the ``ouro``
configurations. The harness hands ``make`` a shape and no configuration, so
the vocabulary is that of the configuration *of this kind* whose model takes
the shape: a kind of its own keeps this family's 4,096-id windows apart from
the six other families' of the same length over other vocabularies (PERF.md
section 7 item 4 (d)). The same seed draws the same ids as those kinds do
over as many rows."""

import json
import os

import numpy as np

KIND = "ouro_tokens"
_CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _vocabulary(shape: tuple) -> int:
    sizes = set()
    for name in sorted(os.listdir(_CONFIGS)):
        with open(os.path.join(_CONFIGS, name)) as f:
            doc = json.load(f)
        if doc.get("inputs", {}).get("kind") == KIND \
                and tuple(doc["model"]["input_shape"]) == tuple(shape):
            sizes.add(int(doc["model"]["num_classes"]))
    if len(sizes) != 1:
        raise ValueError(f"{KIND}: windows of shape {shape} belong to "
                         f"{len(sizes)} vocabularies ({sorted(sizes)})")
    return sizes.pop()


def make(n: int, shape: tuple, seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed % 2 ** 32)
    return rng.randint(0, _vocabulary(shape), size=(n, *shape)).astype(
        np.float64)
