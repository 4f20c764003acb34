"""One float32 instance as an Arrow IPC tensor message: the 0xFF-led frame
that ``api/schema.py decode_instances`` views without a copy. The batch axis
comes first (a bare instance would decode as that many wrong-shaped ones).
Needs ``topology.spout_scheme=raw``, which the traffic file states."""

import numpy as np
import pyarrow as pa


def encode(instance, decimals: int) -> bytes:
    tensor = pa.Tensor.from_numpy(
        np.ascontiguousarray(instance[None], np.float32))
    sink = pa.BufferOutputStream()
    pa.ipc.write_tensor(tensor, sink)
    return sink.getvalue().to_pybytes()
