"""Operations and least bytes of one Ouro step, from shapes: the whole step
(``counts``) and each of its three mechanisms (``kernels``), each count **of
the work the mathematics needs whatever implements it**.

``sizes`` is a configuration's ``published`` group: the published numbers
and, under ``held``, the window's length and the rows of a step (nothing of
the model is cut: every layer, every pass, the whole vocabulary).

The stack of ``num_hidden_layers`` blocks runs ``total_ut_steps`` times over
one set of weights, so every product, every causal pair and every byte of a
block's weights is counted once a layer **and pass**: a pass's 5 GB of
weights cannot stay on the chip until the next, so a step reads them again.

Operations are two per multiply-add of every matrix product; the embedding is
a gather and costs none; the norms (four a block and one a pass), SiLU, the
softmax, the rotary turn and the exit gate's sigmoid run on the vector unit
and are left out, as in ``ops/falcon_h1.py``.

- ``projections``: a token's four ``D x (H * head_dim)`` products.
- ``attention``: a query meets ``(S + 1) / 2`` keys, ``2 * head_dim``
  multiply-adds a pair and head (scores and values); a head reads its own
  keys.
- ``feed_forward``: three ``D x F`` products a token.
- The head reads one row a record, the gate ``total_ut_steps``.

Bytes are the least a step must move between memory and the chip: a block's
weights once a pass, the two ends and the gate once, the ids in (float32)
and the probabilities out (float32). A kernel's: its operands in and its
result out once (the projections' input, q, k, v, the attention's result
and the output projection's; attention's q, k, v and result; the
feed-forward's input and result), in the served type.
"""

import re


def attention_parameters(sizes: dict) -> int:
    return 2 * sizes["hidden_size"] * sizes["head_dim"] * (
        sizes["num_attention_heads"] + sizes["num_key_value_heads"])


def feed_forward_parameters(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def layer_parameters(sizes: dict) -> int:
    """One block: the four projections, the feed-forward, four norms."""
    return (attention_parameters(sizes) + feed_forward_parameters(sizes)
            + 4 * sizes["hidden_size"])


def parameters(sizes: dict) -> int:
    """The whole model: its layers, the embedding, the head, the last norm
    and the exit gate (a weight a channel and a bias)."""
    d = sizes["hidden_size"]
    return (sizes["num_hidden_layers"] * layer_parameters(sizes)
            + 2 * sizes["vocab_size"] * d + d + d + 1)


def applications(sizes: dict) -> int:
    """How often a step runs a block: every layer, every pass."""
    return sizes["num_hidden_layers"] * sizes["total_ut_steps"]


def kernels(sizes: dict, rows: int, bytes_per_value: int) -> dict:
    """``{kernel: {"flops", "bytes"}}`` of one step of ``rows`` windows, each
    kernel summed over every layer and pass."""
    seq = sizes["held"]["sequence_length"]
    times, tokens = applications(sizes), rows * seq
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["head_dim"]
    return {
        "projections": {
            "flops": 2 * times * tokens * attention_parameters(sizes),
            "bytes": times * (attention_parameters(sizes) + tokens * (
                2 * d + 2 * (hq + hkv) * hd)) * bytes_per_value},
        "attention": {
            "flops": times * rows * hq * 4 * hd * (seq * (seq + 1) // 2),
            "bytes": times * tokens * 2 * (hq + hkv) * hd * bytes_per_value},
        "feed_forward": {
            "flops": 2 * times * tokens * 3 * d * f,
            "bytes": times * (3 * d * f + tokens * 2 * d) * bytes_per_value},
    }


def flops_per_row(sizes: dict) -> float:
    """Matrix work of one window."""
    d = sizes["hidden_size"]
    return (sum(k["flops"] for k in kernels(sizes, 1, 2).values())
            + 2 * d * sizes["vocab_size"]  # the head
            + 2 * d * sizes["total_ut_steps"])  # the gate


def rows_per_step(op_names: list, sizes: dict):
    """The windows a compiled program was built for, read off the shapes in
    its operations' names: the commonest ``B`` among ``[B,<window>,<hidden>]``.
    None where no operation names such a shape."""
    seq = sizes["held"]["sequence_length"]
    found = re.findall(rf"\[(\d+),{seq},{sizes['hidden_size']}\]",
                       " ".join(op_names))
    if not found:
        return None
    return int(max(set(found), key=found.count))


def counts(sizes: dict, rows: int, steps: int, bytes_per_value: int) -> dict:
    """``rows`` windows served in ``steps`` executions of the program."""
    d = sizes["hidden_size"]
    ends = 2 * sizes["vocab_size"] * d + 2 * d + 1
    return {
        "flops": rows * flops_per_row(sizes),
        "bytes": steps * (applications(sizes) * layer_parameters(sizes)
                          + ends) * bytes_per_value
        + rows * 4 * (sizes["held"]["sequence_length"] + sizes["vocab_size"]),
    }
