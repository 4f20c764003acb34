"""Windows of token ids, uniform over the rows of the tied matrix that the
configuration's model holds (``model.num_classes``: for ``granite_4_h_small``
rows 0-50,175 of 100,352, the chip's slice), as floats: the instance contract
carries ids so, exactly (under 2^24).

As ``keye_tokens.py`` and ``kimi_k2_tokens.py``, for the ``granite``
configurations. The harness hands ``make`` a shape and no configuration, so
the vocabulary is that of the configuration *of this kind* whose model takes
the shape: a kind of its own keeps this family's 4,096-id windows apart from
``kimi_linear_48b``'s, ``nemotron_3_nano_30b``'s, ``kimi_k2_6``'s and
``solar_open2_250b``'s of the same length over other slices (PERF.md section
7 item 4 (d)). The same seed draws the same ids as those kinds do over as
many rows."""

import json
import os

import numpy as np

KIND = "lfm2_tokens"
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
