"""The reference's wire contract: ``{"instances": [[[[...]]]]}``, one
instance to a record, as UTF-8 text. The values are written with the
decimals the instance was rounded to, so the text is as long as a producer's
would be and parses back to exactly the float32 the reference saw."""

import json

import numpy as np


def encode(instance, decimals: int) -> bytes:
    values = np.round(instance.astype(np.float64), decimals).tolist()
    return json.dumps({"instances": [values]}).encode("utf-8")
