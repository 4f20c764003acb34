"""Operations and least bytes of one Falcon-H1 step, from shapes: the whole
step (``counts``) and each of its three mechanisms (``kernels``), each count
**of the work the mathematics needs whatever implements it**.

``sizes`` is a configuration's ``published`` group: the published numbers and,
under ``held``, what this chip holds of them (which published layers; every
layer is the same parallel block), the window's length, the rows of a step
and the program's own choice of the scan's chunk.

Operations are two per multiply-add of every matrix product; the embedding is
a gather and costs none; RMSNorm, SiLU, softplus, softmax, the short
convolution, the rotary turn, the decays' exponentials and the fourteen
scalars run on the vector unit and are left out, as in ``ops/granite.py``.

- Projections: every token, every held block: the Mamba-2 layer's two and
  the attention layer's four.
- ``ssd_scan``: the chunked form **at the published ``mamba_chunk_size``**
  ``Q`` (128), whatever chunk the program runs, triangles counted half: per
  token and head ``Q/2 * P`` within the chunk, ``Q/2 * N`` a *group* for ``C
  B^T`` (two groups: once for each group's 16 heads), and two ``P * N``
  products with the state (to read it and to write it).
- ``attention``: a query meets ``(S + 1) / 2`` keys, ``2 * head_dim``
  multiply-adds a pair and query head (scores and values).
- ``feed_forward``: three ``D x F`` products a token.

Bytes are the least a step must move between memory and the chip: every
parameter once in the served type, the ids in (float32) and the
probabilities out (float32). A kernel's: its operands in and its result out
once (the scan's ``x``, ``B``, ``C`` and ``y`` in the served type and the
step in float32; attention's ``q`` and result at the query heads' width, its
``k`` and ``v`` at the key heads'; the feed-forward's three matrices once a
layer and a token's input and result in the served type).
"""

import re


def _held(sizes: dict) -> dict:
    held = dict(sizes.get("held", {}))
    held.setdefault("layers", list(range(sizes["num_hidden_layers"])))
    return held


def _mamba_widths(sizes: dict) -> tuple:
    """``(inner, B and C together)`` channels of the Mamba-2 layer."""
    return (sizes["mamba_n_heads"] * sizes["mamba_d_head"],
            2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"])


def mamba_projection_parameters(sizes: dict) -> int:
    d = sizes["hidden_size"]
    inner, bc = _mamba_widths(sizes)
    return d * (2 * inner + bc + sizes["mamba_n_heads"]) + inner * d


def mamba_parameters(sizes: dict) -> int:
    inner, bc = _mamba_widths(sizes)
    return (mamba_projection_parameters(sizes)
            + (sizes["mamba_d_conv"] + 1) * (inner + bc)  # taps and bias
            + 3 * sizes["mamba_n_heads"]  # A_log, dt_bias, D
            + inner)  # the gated norm's scale


def attention_parameters(sizes: dict) -> int:
    return 2 * sizes["hidden_size"] * sizes["head_dim"] * (
        sizes["num_attention_heads"] + sizes["num_key_value_heads"])


def feed_forward_parameters(sizes: dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def layer_parameters(sizes: dict) -> int:
    """One parallel block: both mixers, the feed-forward, two norms."""
    return (mamba_parameters(sizes) + attention_parameters(sizes)
            + feed_forward_parameters(sizes) + 2 * sizes["hidden_size"])


def parameters(sizes: dict) -> int:
    """Parameters this chip holds: its layers, the embedding, the head and
    the last norm."""
    d = sizes["hidden_size"]
    return (len(_held(sizes)["layers"]) * layer_parameters(sizes)
            + 2 * sizes["vocab_size"] * d + d)


def kernels(sizes: dict, rows: int, bytes_per_value: int) -> dict:
    """``{kernel: {"flops", "bytes"}}`` of one step of ``rows`` windows, each
    kernel summed over the held blocks (every block runs all three)."""
    held = _held(sizes)
    layers, seq = len(held["layers"]), held["sequence_length"]
    tokens = rows * seq
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    heads, p = sizes["mamba_n_heads"], sizes["mamba_d_head"]
    groups, n = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    q = sizes["mamba_chunk_size"]
    inner, bc = _mamba_widths(sizes)
    scan_macs = heads * (q // 2 * p + 2 * p * n) + groups * (q // 2 * n)
    hq, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes["head_dim"]
    return {
        "ssd_scan": {
            "flops": 2 * layers * tokens * scan_macs,
            "bytes": layers * tokens * ((2 * inner + bc) * bytes_per_value
                                        + 4 * heads)},
        "attention": {
            "flops": layers * rows * hq * 4 * hd * (seq * (seq + 1) // 2),
            "bytes": layers * tokens * 2 * (hq + hkv) * hd
            * bytes_per_value},
        "feed_forward": {
            "flops": 2 * layers * tokens * 3 * d * f,
            "bytes": layers * (3 * d * f + tokens * 2 * d)
            * bytes_per_value},
    }


def flops_per_row(sizes: dict) -> float:
    """Matrix work of one window."""
    held = _held(sizes)
    seq = held["sequence_length"]
    per_token = 2 * len(held["layers"]) * (
        mamba_projection_parameters(sizes) + attention_parameters(sizes))
    parts = kernels(sizes, 1, 2)
    return (seq * per_token + sum(k["flops"] for k in parts.values())
            + 2 * sizes["hidden_size"] * sizes["vocab_size"])  # the head


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
        + rows * 4 * (held["sequence_length"] + sizes["vocab_size"]),
    }
