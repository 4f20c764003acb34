"""Operations and least bytes of one EvaByte step, from shapes: the whole step
(``counts``) and its two mechanisms (``kernels``).

``sizes`` is a configuration's ``published`` group: the published numbers and,
under ``held``, what this chip holds of them (layers), the window's length
and the rows of a step.

Operations are two per multiply-add of every matrix product; the embedding is
a gather and costs none; RMSNorm, SiLU, softmax, the rotary turn and the two
poolings of a chunk (a product and a sum a channel, 16 positions at a time)
run on the vector unit and are left out, as in ``ops/vit.py`` and
``ops/minicpm_sala.py``. **The work is the model's, whatever implements it.**

- Projections: every position, every held layer: the mixer's ``q``, ``k``,
  ``v`` and ``o`` (four square products), the SwiGLU's three.
- The attention (``eva_attention``): a query of each head meets the keys of
  its own window up to its position and one summary for every chunk of every
  earlier window, ``2 * head_dim`` multiply-adds a pair read (the score and
  the value product).
- The summaries (``eva_chunks``): no matrix work; bound by its bytes.
- The head: the last position's norm against ``num_pred_heads * vocab_size``
  columns.

Bytes are the least a step must move between memory and the chip: every
parameter once in the served type, the ids in (float32) and the probabilities
out (float32). A kernel's: its operands in and its result out once in the
served type: the attention's ``q``, ``k``, ``v``, the summaries and the
result; the summaries' ``k`` and ``v`` in and ``kbar`` and ``vbar`` out.
"""

import re


def _held(sizes: dict) -> dict:
    held = dict(sizes.get("held", {}))
    held.setdefault("num_hidden_layers", sizes["num_hidden_layers"])
    return held


def head_dim(sizes: dict) -> int:
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def parameters(sizes: dict) -> int:
    """Parameters this chip holds."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    layer = 4 * d * d + 3 * d * f + 2 * d \
        + 2 * sizes["num_attention_heads"] * head_dim(sizes)
    ends = sizes["vocab_size"] * d + d \
        + d * sizes["num_pred_heads"] * sizes["vocab_size"]
    return _held(sizes)["num_hidden_layers"] * layer + ends


def reads(sizes: dict) -> tuple:
    """``(keys, summaries)`` one head's queries read in one window of the
    traffic, summed over its positions: the keys of a query's own attention
    window up to itself, and the chunks of every earlier one."""
    seq = _held(sizes)["sequence_length"]
    window, chunk = sizes["window_size"], sizes["chunk_size"]
    keys = sum(t % window + 1 for t in range(seq))
    summaries = sum(t // window * (window // chunk) for t in range(seq))
    return keys, summaries


def kernels(sizes: dict, rows: int, bytes_per_value: int, **_) -> dict:
    """``{kernel: {"flops", "bytes"}}`` of one step of ``rows`` windows, each
    kernel summed over the held layers."""
    held = _held(sizes)
    layers, seq = held["num_hidden_layers"], held["sequence_length"]
    heads, d = sizes["num_attention_heads"], head_dim(sizes)
    wide = rows * seq * heads * d  # q, k, v or the result, in values
    pooled = wide // sizes["chunk_size"]  # kbar or vbar
    return {
        "eva_attention": {
            "flops": 2 * layers * rows * heads * 2 * d * sum(reads(sizes)),
            "bytes": layers * (4 * wide + 2 * pooled) * bytes_per_value},
        "eva_chunks": {
            "flops": 0,
            "bytes": layers * (2 * wide + 2 * pooled) * bytes_per_value},
    }


def flops_per_row(sizes: dict) -> float:
    """Matrix work of one window."""
    held = _held(sizes)
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    per_token = 2 * held["num_hidden_layers"] * (4 * d * d + 3 * d * f)
    return (held["sequence_length"] * per_token
            + kernels(sizes, 1, 2)["eva_attention"]["flops"]
            + 2 * d * sizes["num_pred_heads"] * sizes["vocab_size"])


def rows_per_step(op_names: list, sizes: dict):
    """The windows a compiled program was built for, read off the shapes in
    its operations' names: the commonest ``B`` among ``[B,<window>,<hidden>]``.
    None where no operation names such a shape."""
    seq = _held(sizes)["sequence_length"]
    found = re.findall(rf"\[(\d+),{seq},{sizes['hidden_size']}\]",
                       " ".join(op_names))
    if not found:
        return None
    return int(max(set(found), key=found.count))


def counts(sizes: dict, rows: int, steps: int, bytes_per_value: int) -> dict:
    """``rows`` windows served in ``steps`` executions of the program."""
    held = _held(sizes)
    return {
        "flops": rows * flops_per_row(sizes),
        "bytes": steps * parameters(sizes) * bytes_per_value
        + rows * 4 * (held["sequence_length"]
                      + sizes["num_pred_heads"] * sizes["vocab_size"]),
    }
